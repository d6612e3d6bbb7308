"""sig_prep_s (s): the significance runner's set-up, a part of set-up.

Source: ``prep_s`` of the program's ``sig/unit`` span: the bucket plan,
the targets' futures and the seeded library permutation, paid once per
runner (the first unit it runs reports it).  With several units, the
first to start.
"""
import program_spans

SPAN = "sig/unit"


def read(w):
    units = [s for s in program_spans.named(w, SPAN) if "prep_s" in s[3]]
    if not units:
        return None
    return float(min(units, key=lambda s: s[1])[3]["prep_s"])
