"""``op: write_full`` is the closed loop (``windows/closed_loop.py``)."""

from windows.closed_loop import Window  # noqa: F401
