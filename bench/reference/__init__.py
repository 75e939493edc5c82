"""Plain float32 references of the benchmark's model families, one module
per family named by a configuration's ``reference`` key. Each gives the
parameter tree (`spec`) and the next-token loss of one row (`row_loss`),
and imports nothing of the program."""
