"""The control of "How correct is decided": the plain reference put in
the program's place, computed one precision step below what the
configuration states. It has to come out NOT correct.

    python3 benchmarks/tools/control.py --workload <name> --seed <n> --seconds <s>

A serving cell runs as usual with a short window, then reads, at each
position of the same prompts and served tokens, the gap of the token
the lower precision puts first. A training cell needs no window: the
reference follows the steps twice, in float32 and in the lower
precision, and the two are compared as program and reference are.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmarks import run
    from benchmarks.lib import check, manifest, traffic
    from benchmarks.references.common import CONTROL_OF
    argv = list(sys.argv[1:] if argv is None else argv)
    forced = None
    if "--control" in argv:         # try another control than the file's
        i = argv.index("--control")
        forced = argv[i + 1]
        del argv[i:i + 2]
    args = run.parse(argv)
    man = manifest.load()
    cell = manifest.cell(man, args.workload)
    config = manifest.config_of(man, cell)
    control = forced or config.get("control") or \
        CONTROL_OF[config["compute_precision"]]
    if config["job"] != "train_fit":
        return run.main(argv, control=control)
    import jax  # noqa: F401
    from benchmarks.lib import device
    from benchmarks.lib.jobs import train_fit
    device.gate(cell["chips"])
    mix = traffic.load_mix(cell["traffic"])
    ctx = {"config": config, "seed": args.seed, "chips": cell["chips"]}
    rows = mix["rows_per_chip"] * cell["chips"]
    family = manifest.family(config)
    sound = train_fit.follow_reference(ctx, family, rows, mix["seq"],
                                       "highest")
    low = train_fit.follow_reference(ctx, family, rows, mix["seq"], control)
    numbers = check.training_numbers(low, sound,
                                     check.limits_of(cell["name"]))
    for what, value, limit, ok in numbers:
        print(f"control ({control}) compared: {what}: {value:.6g} (limit "
              f"{limit:.6g}) {'ok' if ok else 'NOT CORRECT'}", flush=True)
    print(json.dumps({"control": control, "seed": args.seed,
                      "correct": all(ok for *_, ok in numbers),
                      "numbers": [[w, v, l] for w, v, l, _ in numbers]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
