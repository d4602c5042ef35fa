"""The sequence model (JoyAI-LLM-Flash, DeepSeek-V3 family): imported
only when ``MODEL.NAME`` selects it (``eksml_tpu.models.build_model``).
"""

from eksml_tpu.models.lm.model import (  # noqa: F401
    COUNTER_SPANS, JoyAIFlash, decay_mask)
