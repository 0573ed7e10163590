"""Harness clock: first DDL statement -> Initial barrier collected (frontend/session.py, binder.py, plan/build.py)."""

LAYER = "SQL front end"
UNIT = "s"
MOVES = "setup_s"
NEEDS_TRACE = False


def read(run):
    return run["deploy_s"]
