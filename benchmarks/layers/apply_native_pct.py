"""Share of the window's group batches that the state machine's ONE native
call committed (`apply.native_txns` over it + `apply.python_txns`;
runtime/db.py `_apply_run`, one `count()` a run from what `_apply_group`
returns).  A batch the native call gave back and the Python loop then ran
counts as python.  `None` on a program without the counters, or where the
window applied nothing.
"""
from lib import stats


def read(before, after, client, trace):
    native = stats.delta(before["engine"], after["engine"],
                         "apply.native_txns")
    python = stats.delta(before["engine"], after["engine"],
                         "apply.python_txns")
    if native is None or python is None or not native + python:
        return None
    return 100.0 * native / (native + python)
