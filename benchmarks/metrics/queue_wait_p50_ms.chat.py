"""`submit` -> `prefill_start` of the requests whose timelines the
harness read through the window, median."""
import statistics


def read(obs):
    waits = [d["phases"]["queue_wait_s"] for d in
             obs.get("timelines", {}).values() if d.get("phases")]
    return 1e3 * statistics.median(waits) if waits else None
