"""User queries answered inside the window (scored or filtered by the
serving stack, or all their checks answered by the MCT wrapper), over
the window's length (host clock)."""


def read(run):
    return len(run.answered_in_window()) / run.seconds
