"""Plain float32 references the checks compare the program with."""
