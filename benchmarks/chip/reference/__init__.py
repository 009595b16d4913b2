"""Plain float64 references of one iteration of each format, and the
comparisons that decide `correct`.  They import nothing of the program."""
