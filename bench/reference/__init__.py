"""Plain references the output check compares the program with."""
