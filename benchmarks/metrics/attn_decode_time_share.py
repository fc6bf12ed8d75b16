"""Device time under the program's scopes ``swa`` + ``attn_full`` (the two
kinds' attention: the paged kernels and their page writes) in the decode-chain
program over that program's own device time in the traced window."""

from benchmarks.lib import two_width


def read(run, trace):
    return two_width.attention_share_of_chains(run, trace)
