"""As `lane_occupancy.doc`, in the long-chat cell."""
from benchmarks.lib import manifest

read = manifest.reader("lane_occupancy.doc")
