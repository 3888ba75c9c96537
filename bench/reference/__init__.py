"""Plain references the benchmark compares the program with; they import
nothing of the program."""
