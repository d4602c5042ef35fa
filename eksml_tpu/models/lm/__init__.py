"""The three sequence models: JoyAI-LLM-Flash (``model.py``,
DeepSeek-V3 family), Ouro (``ouro.py``, LoopLM) and Laguna
(``laguna.py``: window and full attention mixed over grouped key-value
heads, JoyAI's expert layer), each imported only when ``MODEL.NAME``
selects it (``eksml_tpu.models.build_model``).
"""

from eksml_tpu.models.lm.model import (  # noqa: F401
    COUNTER_SPANS, JoyAIFlash, decay_mask)
