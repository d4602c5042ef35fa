"""The two sequence models: JoyAI-LLM-Flash (``model.py``, DeepSeek-V3
family) and Ouro (``ouro.py``, LoopLM), each imported only when
``MODEL.NAME`` selects it (``eksml_tpu.models.build_model``).
"""

from eksml_tpu.models.lm.model import (  # noqa: F401
    COUNTER_SPANS, JoyAIFlash, decay_mask)
