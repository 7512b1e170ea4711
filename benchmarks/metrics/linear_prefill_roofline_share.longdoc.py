"""The linear layers' prefill form's share of its roofline. The least
time is `costs_sala.linear_prefill_floor_s` over the real tokens of the
traced windows (`serving/prefill/window` spans): the larger of the
bytes floor (q, k, v in and o out) and of the recurrence's operations,
whatever chunk the program picks; the time taken is the device seconds
under the scope `fstpu_lightning_prefill` over the traced window,
scaled to the windows whose spans were seen."""
from benchmarks.lib import costs_sala, trace_lines


def read(obs):
    spans = trace_lines.window_spans(obs)
    runs = trace_lines.module_runs(obs, trace_lines.WINDOW)
    taken = trace_lines.scope_seconds(obs, "fstpu_lightning_prefill")
    if not spans or not runs or not taken:
        return None
    needed = costs_sala.linear_prefill_floor_s(
        sum(n for _, n in spans), obs["config"], obs["peaks"])
    return 100.0 * needed / (taken * len(spans) / len(runs))
