"""Model FLOPs per step times steps over the window, the cell's chips
and the bf16 peak: ``mfu.train``'s count, read in the tensor-parallel
cell."""
from chipbench.bench import load_reader

read = load_reader("mfu.train")
