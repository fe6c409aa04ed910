"""p90 over the requests due in the window of (the dispatch of the
request's admission, ``Request.t_admit``) - (its due time); a request not
admitted by the close counts with the close."""

from bench import readers


def read(facts):
    p90 = readers.percentile(facts.get("queue_waits_s", []), 90)
    return None if p90 is None else 1e3 * p90
