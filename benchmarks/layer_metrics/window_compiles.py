"""Backend compilations inside the measured window; should read 0."""


def read(obs):
    return obs["compile_window"]["count"]
