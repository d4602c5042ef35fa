"""Plain float32 reference of the training step (no import of the program)."""
