"""`check.reopen_and_compare`'s own `reopen_steps.session_recover_s`: the store is open -> `Session.recover()` has returned (the catalog re-read, the dictionary log replayed, the MV's executors rebuilt and every stateful executor's `recover()` run: state tables scanned, device state rebuilt, the programs that takes loaded or compiled). The part of `recovery_s` the executors own; the rest is `store_open_s` and the first checkpoint. Nothing to read in a cell without a timed recovery."""

LAYER = "stateful executors"
UNIT = "s"
MOVES = "recovery_s"
NEEDS_TRACE = False


def read(run):
    return (run["check"].get("reopen_steps") or {}).get("session_recover_s")
