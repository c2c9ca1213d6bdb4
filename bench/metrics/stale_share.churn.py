"""Share of the window's answers served at a version behind the head,
in %, from the serve loop's own record (`ServeReport` staleness)."""


def read(run):
    if not run.answered:
        return None
    return 100.0 * float((run.staleness > 0).mean())
