"""Plain references and generators kept with the benchmark; they import
nothing of the program."""
