"""The plain reference of the sequence task (JoyAI-LLM-Flash on one
chip's share of an EP16 deployment): ``model.py`` (weights from the
seed, forward, both losses) and ``train.py`` (gradients, clip, AdamW)."""
