"""TAQA: mean per query of the final scan's blocks over the table's
blocks, percent."""

from chipbench import layers


def read(w):
    return layers.sampled_block_pct(w)
