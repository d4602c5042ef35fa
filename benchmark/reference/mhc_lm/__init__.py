"""The plain reference of the hyper-connected task (Xing4.0): the
model's loss (``model.py``) and its first training steps
(``train.py``).  Takes no array and no code from the program."""
