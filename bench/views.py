"""The forward dispatches of one view in a run's spans: the
``sweep.execute`` spans whose ``view`` attribute names the forward that ran
(``segment``, ``pallas``, ``sparse`` or ``sparse_pallas``) and that record
their ``trips``.  A program whose spans carry neither has none, and the
readers of the view's metrics (``metrics/*.dense.py``) then read nothing.
"""

from __future__ import annotations


def dispatches(events: list, view: str) -> list:
    """The ``sweep.execute`` events of ``view``, with ``trips``."""
    return [e for e in events if e.name == "sweep.execute" and e.args
            and e.args.get("view") == view and "trips" in e.args]
