"""Blocks of the lanes' table rows a paged decode kernel walks (up to
each lane's physical cursor; a free lane's one null block) over every
block the rows name (lanes x table width): counter deltas over the
window. The share of the table that still costs a step, a fetch and a
matmul; reported where a Mosaic kernel walks a lane's live blocks (the
paged decode kernel, the folded kernel)."""
from benchmarks.lib import serving

read = serving.decode_live_block_share
