"""Share of the traced window in which device 0 ran nothing while some
``mct.encode`` span was open and no ``mct.compile`` was
(``bench/idle_split.py``), in %."""
from bench import idle_split


def read(run):
    parts = idle_split.shares(run.trace)
    return None if parts is None else parts["encode"]
