"""Percent of the answered requests whose action runs some step at
fp64 (`action_names` of the answers): the rows the policy routes
through the carrier's fp64 arithmetic."""


def read(rec):
    d = [("fp64" in (a["action_names"] or ())) for a in rec["answers"]]
    return 100.0 * sum(d) / len(d) if d else None
