"""`lfm2_shortconv_ms_per_tick` — layer: kernels. Device time of the gated
short-convolution operators a decode execution, found by the scopes the mixer
opens (`shortconv.project`, `shortconv.conv`, `shortconv.out`;
program_scopes.py: two weight products and the chain of small operations of
the window between them, in XLA; one operator a conv layer). Should move
`itl_p90_ms`.
"""
import program_scopes


@program_scopes.reader
def read(obs, run):
    return program_scopes.decode_ms(
        obs, run, {"shortconv.project", "shortconv.conv", "shortconv.out"})
