"""Plain references, one module per model family, named by a config's
``reference`` key.  They import nothing of the program."""
