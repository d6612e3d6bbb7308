"""host_slack_share (%): time the host spent waiting for the chip, over
the window.

Source: the program's ``phase2/device_wait`` spans (the drain's
``block_until_ready`` on the oldest chunk in flight), clipped to the
window.  It is the host's slack: time it had nothing to do but wait.
Near 0, the host path sets the pace and a faster kernel would not show
end to end.
"""
import program_spans

SPAN = "phase2/device_wait"


def read(w):
    return program_spans.window_share(w, SPAN)
