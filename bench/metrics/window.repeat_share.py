"""Share of the window's submissions, in %, that repeat a user query
submitted before in the same run: a closed loop's clients wrap round
their pool once the program has been offered all of it. 0 while the pool
lasts the window; where it is above 0, later laps repeat queries and
check counts, and a gain from having seen them before is not a gain."""


def read(run):
    if not run.query_of:
        return None
    return 100.0 * (1 - len(set(run.query_of.values())) / len(run.query_of))
