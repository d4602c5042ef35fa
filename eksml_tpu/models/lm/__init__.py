"""The four sequence models: JoyAI-LLM-Flash (``model.py``,
DeepSeek-V3 family), Ouro (``ouro.py``, LoopLM), Laguna (``laguna.py``:
window and full attention mixed over grouped key-value heads, JoyAI's
expert layer) and Xing4.0 (``xing.py``: JoyAI's block on four residual
streams mixed by the hyper-connections of ``mhc.py``), each imported
only when ``MODEL.NAME`` selects it (``eksml_tpu.models.build_model``).
"""

from eksml_tpu.models.lm.model import (  # noqa: F401
    COUNTER_SPANS, JoyAIFlash, decay_mask)
