"""phase2_first_dispatch_s (s): the first chunk's dispatch in the work
unit, a part of set-up.

Source: ``first_dispatch_s`` of the program's ``phase2/unit`` span: the
wall time of the first chunk's jitted calls, which trace, lower and
compile the chunk function or load it from the compile cache.  A fleet
worker pays it on every unit.
"""
import program_spans


def read(w):
    return program_spans.unit_attr(w, "first_dispatch_s")
