"""What a PADDED prompt token costs the device: device seconds of the
prefill programs' runs on the trace's module line (`jit_window_fn` for
prompts prefilled in windows, `jit_prefill_fn` for whole prompts) over
the tokens those runs were wide (the window; the `bucket` attribute of
the `serving/prefill` span that dispatched the run), whatever mix of
widths the traced seconds held."""
from benchmarks.lib import trace_lines

read = trace_lines.prefill_us_per_padded_token
