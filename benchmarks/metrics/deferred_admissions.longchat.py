"""As `deferred_admissions.doc`, in the long-chat cell."""
from benchmarks.lib import manifest

read = manifest.reader("deferred_admissions.doc")
