"""General code of the chip benchmark: found by name, driven by data.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``; the modules here read them.
"""
