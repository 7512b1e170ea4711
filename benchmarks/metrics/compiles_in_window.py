"""Programs built inside the window; anything but 0 makes the run
incorrect."""


def read(obs):
    return obs["compiles_in_window"]
