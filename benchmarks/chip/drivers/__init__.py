"""One driver per entry point of the program that a window drives."""
