"""Profile attribution: compiled-HLO cost → named model components.

See attribution.py for the engine; tools/trace_summary.py and
predict.py are the consumers.
"""

from eksml_tpu.profiling.attribution import (FLOPS_PER_BYTE,  # noqa: F401
                                             HloAttribution,
                                             attribution_map,
                                             component_table,
                                             is_collective_opcode,
                                             parse_hlo,
                                             resolve_component,
                                             write_attribution_artifact)

__all__ = [
    "HloAttribution", "attribution_map", "component_table",
    "parse_hlo", "resolve_component", "write_attribution_artifact",
    "FLOPS_PER_BYTE", "is_collective_opcode",
]
