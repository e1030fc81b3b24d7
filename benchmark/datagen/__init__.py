"""One file a table: ``make(rows, rng, parent=None) -> {column: ndarray}``
and ``TYPES``, the engine type of each column in order (harness/frames.py
reads the spellings).  Copied from bench.py's generators; the seed comes
from ``--seed`` and the row count from the configuration file."""
