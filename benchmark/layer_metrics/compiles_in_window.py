"""`compiles_in_window` — layer: compile cache. jax `monitoring`
`backend_compile_duration` events between the window's opening and its close
(program counter; an XLA compile or a persistent-cache load). Must be 0. Should move `train_images_per_s`.
"""


def read(obs, run):
    return obs["compiles_in_window"]
