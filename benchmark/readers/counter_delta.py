"""How far a program counter moved over the window."""


def read(obs, params):
    return obs.delta(params["counter"])
