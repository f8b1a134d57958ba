"""Plain float32 references, one module per model family. They import
nothing of the program and take nothing it has made: tables are drawn here
from the seed, the batches are the generator's own rows."""
