"""`submit` -> `enqueued` (stamped once the scheduler's lock is held) of
the requests whose timelines `queue_wait_p50_ms.chat` reads, median: the
part of that queue wait spent on the lock."""
import statistics


def read(obs):
    waits = [d["phases"]["lock_wait_s"] for d in
             obs.get("timelines", {}).values()
             if d.get("phases") and "lock_wait_s" in d["phases"]]
    return 1e3 * statistics.median(waits) if waits else None
