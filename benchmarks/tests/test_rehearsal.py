"""Each cell end to end at a tiny size on the CPU; the controls; the
timed path broken underneath.

The harness runs on the chip or not at all, so these tests lift its
gate themselves (no option of the harness does) and stand a nominal
peak in for the CPU. What they prove is paths, arguments and control
flow, and that `correct` can come out false. No time here is a result.
"""

import importlib

import pytest

from benchmarks import run
from benchmarks.lib import check, device, manifest
from benchmarks.tests import expected, tiny

MAN = manifest.load()
CELLS = {
    "mistral_doc_saturated": (tiny.mistral, tiny.doc, tiny.SERVE_LIMITS),
    "mistral_chat_steady": (tiny.mistral, tiny.chat, tiny.SERVE_LIMITS),
    "wenzhong_pretrain_1chip": (tiny.gpt2, tiny.pretrain, tiny.TRAIN_LIMITS),
}
# a cell a later PR adds by entries alone: its files are here already,
# and the four virtual devices stand in for the four-chip host
FSDP4 = {"name": "wenzhong_pretrain_fsdp4",
         "config": "wenzhong-gpt2-3.5b-fsdp4",
         "traffic": "pretrain_packed_1k", "chips": 4}


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setattr(device, "REQUIRED_PLATFORM", "cpu")
    monkeypatch.setattr(device, "peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def execute(cell, trace, seed=2 ** 31 + 11, seconds=2.0, control=None):
    config, mix, limits = CELLS[cell]
    return run.execute(MAN, manifest.cell(MAN, cell), config(), mix(),
                       limits, seed, seconds, trace, control)


@pytest.mark.parametrize("cell", list(CELLS))
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end_and_is_correct(cell, trace):
    result = execute(cell, trace)
    assert result["correct"] is True, result
    assert result["failed"] == 0 and result["attempted"] > 0
    e2e, per = manifest.metrics_of(MAN, cell)
    if not trace:
        assert set(result["metrics"]) == {m["name"] for m in e2e}
        assert all(v["value"] > 0 for v in result["metrics"].values())
    else:
        # readers of device time find no device plane on a CPU and
        # return nothing; the counters and host clocks are all there
        assert expected.counters(MAN, cell) <= set(result["metrics"]) <= \
            {m["name"] for m in per}
        assert {"compile_s", "compiles_in_window"} <= set(result["metrics"])
        assert result["metrics"]["compiles_in_window"]["value"] == 0
        assert {"busy_s", "window_s"} <= set(result["device"])
    assert result["device"]["platform"] == "cpu"


def test_the_four_chip_training_cell_runs_on_four_virtual_devices():
    man = dict(MAN, workloads=MAN["workloads"] + [FSDP4])
    for group in ("end_to_end", "per_layer"):
        man[group] = [dict(m, workloads=m["workloads"] + [FSDP4["name"]])
                      if "wenzhong_pretrain_1chip" in m.get("workloads", ())
                      else m for m in MAN[group]]
    result = run.execute(man, FSDP4, tiny.gpt2(4), tiny.pretrain(),
                         tiny.TRAIN_LIMITS, 7, 2.0, False)
    assert result["correct"] is True, result
    assert result["device"]["count"] == 4
    assert result["metrics"]["train_tokens_per_s_chip"]["value"] > 0


def test_the_gate_refuses_the_cpu_and_prints_no_result(monkeypatch, capsys):
    monkeypatch.setattr(device, "REQUIRED_PLATFORM", "tpu")
    with pytest.raises(SystemExit) as e:
        execute("mistral_doc_saturated", False)
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


def test_the_gate_refuses_fewer_chips_than_the_cell_asks_for():
    with pytest.raises(SystemExit):
        device.gate(64)


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from fengshen_tpu.serving import engine as engine_module
    real = engine_module._select_token

    def altered(logits, *args, **kw):
        return (real(logits, *args, **kw) + 1) % logits.shape[-1]
    monkeypatch.setattr(engine_module, "_select_token", altered)
    result = execute("mistral_doc_saturated", False)
    assert result["correct"] is False
    assert result["failed"] == 0        # every request was still answered


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from fengshen_tpu.trainer.train_state import TrainState
    monkeypatch.setattr(
        TrainState, "apply_gradients",
        lambda self, grads: self.replace(step=self.step + 1))
    result = execute("wenzhong_pretrain_1chip", False)
    assert result["correct"] is False


def test_serving_control_in_int8_fails_where_the_program_passes():
    result = execute("mistral_chat_steady", False, control="int8")
    assert result["correct"] is True


def test_training_control_in_fp8_fails_one_number():
    from benchmarks.lib.jobs import train_fit
    config, mix = tiny.gpt2(), tiny.pretrain()
    ctx = {"config": config, "seed": 5, "chips": 1}
    family = manifest.family(config)
    sound = train_fit.follow_reference(ctx, family, 2, mix["seq"], "highest")
    assert config["control"] == "fp8"
    low = train_fit.follow_reference(ctx, family, 2, mix["seq"],
                                     config["control"])
    numbers = check.training_numbers(low, sound, tiny.TRAIN_LIMITS)
    assert not all(ok for *_, ok in numbers), numbers
    again = train_fit.follow_reference(ctx, family, 2, mix["seq"], "highest")
    assert all(ok for *_, ok in
               check.training_numbers(again, sound, tiny.TRAIN_LIMITS))


def test_references_import_nothing_of_the_program():
    import sys
    for name in ("gpt2", "mistral", "common"):
        module = importlib.import_module("benchmarks.references." + name)
        with open(module.__file__) as f:
            assert "fengshen_tpu" not in f.read()
    assert "benchmarks.references.gpt2" in sys.modules
