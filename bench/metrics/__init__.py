"""Per-layer metrics, one module each, named as in ``BENCHMARK.json``.

Each module's ``read(run)`` returns the metric's value, or None where the
run gave it nothing to read.  ``run`` holds the reduced trace
(``summary``), the configuration (``cfg``), the cell's engine settings
(``engine``), the engine's counter deltas over the traced window
(``stats``), the context length of each token decoded while the trace ran
(``decode_contexts``), the prompt lengths of the requests whose first token
arrived then (``prompt_lengths``) and the chip's peaks (``peaks``).
"""


def module(run, name):
    """The reduced trace's entry for the program ``name``, if it ran."""
    m = run["summary"]["modules"].get(name)
    return m if m and m["count"] else None
