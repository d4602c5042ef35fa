"""The plain reference of the looped-stack task (Ouro, LoopLM): the
model's losses (``model.py``) and its first training steps
(``train.py``).  Takes no array and no code from the program."""
