"""The benchmark's harness: everything a run needs that is not data.

The yardstick lives here (traffic generation, the reduction of traces to
metrics, the operation and byte counts, the plain reference and the
comparison that decides ``correct``); the cells' data lives beside it in
``configs/``, ``traffic/``, ``metrics/`` and ``peaks.json``.
"""
