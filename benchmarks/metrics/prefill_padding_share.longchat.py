"""As `prefill_padding_share.doc`, in the long-chat cell."""
from benchmarks.lib import manifest

read = manifest.reader("prefill_padding_share.doc")
