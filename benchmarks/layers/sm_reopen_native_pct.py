"""Share of the window's reopens (a use that found its group's handle
closed) whose set-up ONE native call on the new connection's handle did,
in % (100 Δ`sm.native_reopens` / (Δ`sm.native_reopens` +
Δ`sm.python_reopens`); models/store.py `_pin` counts both under its lock,
read at the scrape as gauges).  A reopen of a machine whose last handle
was not verified, or whose new handle names another file, counts as
python.  `None` where nothing was reopened in the window, or the program
counts neither.
"""
from lib import stats


def read(before, after, client, trace):
    native = stats.delta(before["engine"], after["engine"],
                         "sm.native_reopens")
    python = stats.delta(before["engine"], after["engine"],
                         "sm.python_reopens")
    if native is None or python is None or not native + python:
        return None
    return 100.0 * native / (native + python)
