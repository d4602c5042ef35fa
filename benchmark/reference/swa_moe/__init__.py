"""The plain reference of the window-and-experts task (Laguna): the
model's loss (``model.py``) and its first training steps
(``train.py``).  Takes no array and no code from the program."""
