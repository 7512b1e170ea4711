"""As `kv_blocks_peak_share.doc`, in the long-chat cell."""
from benchmarks.lib import manifest

read = manifest.reader("kv_blocks_peak_share.doc")
