"""As `decode_live_block_share.serve`, in the open loop: ~4 live lanes of
32, so most rows are a free lane's one null block."""
from benchmarks.lib import serving

read = serving.decode_live_block_share
