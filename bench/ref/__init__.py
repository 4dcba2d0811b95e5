"""Plain reference of the design scorer, independent of the program."""
