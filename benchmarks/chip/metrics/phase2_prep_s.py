"""phase2_prep_s (s): the work unit's preparation, a part of set-up.

Source: ``prep_s`` of the program's ``phase2/unit`` span: the bucket
plan, the futures gathered into bucket order on the host and put on the
device.  A fleet worker pays it on every unit.
"""
import program_spans


def read(w):
    return program_spans.unit_attr(w, "prep_s")
