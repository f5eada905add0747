"""One of the worker's cumulative counters inside the window
(``runfiles.counter_records``): ``params["how"]`` = ``growth`` is its
growth between the window's first and last ``counter`` record, optionally
over another counter's growth (``over``), and ``last`` its value in the
last record; both times ``params["scale"]``.

The arithmetic is ``counter_delta``'s and ``counter_last``'s, called by
path, not copied.  This file exists because
``tests/benchmark/test_host_spans_and_counters.py`` counts the metrics that
name those two readers (ten, PR 24's), and a PR that adds a cell may not
edit a file the benchmark has; a ``benchmark`` PR can point the ``.ex4``
metrics at them and delete this.
"""

import os

import resolve

_HERE = os.path.dirname(os.path.abspath(__file__))
_READERS = {"growth": "counter_delta", "last": "counter_last"}


def read(ctx: dict, params: dict):
    reader = resolve.load_module(os.path.join(_HERE, _READERS[params["how"]] + ".py"))
    return reader.read(ctx, params)
