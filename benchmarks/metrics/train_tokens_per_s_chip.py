"""Tokens consumed by `Trainer.fit` steps inside the window, over the
window's seconds and the chips."""


def read(obs):
    lo, hi = obs["window"]
    return obs["steps_in_window"] * obs["tokens_per_step"] / (hi - lo) \
        / obs["chips"]
