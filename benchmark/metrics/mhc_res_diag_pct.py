"""model - eksml_tpu/models/lm: the share each residual stream keeps of
itself through the hyper-connections, in the hyper-connected task's
cell: mean of ``trace(H_res) / n`` over the step's sublayers and
positions (the step's ``mhc_res_diag`` counter), x 100, mean over the
window's ``mhc`` spans.  25 is a uniform mix of four streams, 100 no
mixing.  **Informative**: it says how much the streams mix, not how
fast the step runs; ``moves`` and ``better`` are the manifest's form."""

from benchmark.metrics.mhc_sinkhorn_err import span_values


def read(ctx):
    values = span_values(ctx, "mhc_res_diag")
    return 100.0 * sum(values) / len(values) if values else None
