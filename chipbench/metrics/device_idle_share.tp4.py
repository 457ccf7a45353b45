"""Share of the traced window in which no operation ran on a chip, the
mean over the cell's chips."""
from chipbench.readers import idle_share as read  # noqa: F401
