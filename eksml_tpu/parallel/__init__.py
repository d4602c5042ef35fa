"""Parallelism layer: mesh, distributed init, collectives.

Replaces the reference's NCCL + Horovod + OpenMPI stack
(SURVEY.md §5.8): rendezvous via JobSet stable DNS +
``jax.distributed.initialize`` instead of mpirun/kubectl-delivery
(charts/maskrcnn/templates/maskrcnn.yaml:47-55); collectives via XLA
over ICI/DCN instead of NCCL rings (values.yaml:26-28), combined by
the compiler (HOROVOD_FUSION_THRESHOLD, values.yaml:24-25, has no knob
here).  SPMD inverts the launcher-pushes-ranks model:
every host runs the same program, the Mesh defines parallelism.
"""

from eksml_tpu.parallel.mesh import (  # noqa: F401
    build_mesh, validate_topology, batch_sharding, replicated_sharding,
    slice_groups, topology_label)
from eksml_tpu.parallel.distributed import (  # noqa: F401
    initialize_from_env, process_count, process_index)
from eksml_tpu.parallel.collectives import (  # noqa: F401
    cross_host_sum, param_fingerprint, warm_mesh_collectives)
from eksml_tpu.parallel.sharding import (  # noqa: F401
    ShardingPlan, match_partition_rules, plan_mesh,
    tree_bytes_per_device)
from eksml_tpu.parallel.topology import current_topology  # noqa: F401
