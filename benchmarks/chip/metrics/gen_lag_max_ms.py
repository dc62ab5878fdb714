"""Load generator: the latest a request was submitted after its due
time.  The one host thread submits between engine steps, so this is at
most about one step; a larger value means the generator, not the
server, set the time to first token."""


def read(run):
    recs = run.window.recs
    if not recs:
        return None
    return max(r.submitted - r.due for r in recs) * 1e3
