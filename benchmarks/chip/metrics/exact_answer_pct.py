"""TAQA: answered queries whose report has a fallback (an exact answer),
over all answered queries, percent."""

from chipbench import exactpath


def read(w):
    return exactpath.answer_pct(w)
