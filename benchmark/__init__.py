"""The benchmark: the yardstick, kept apart from the program."""
