"""raft_tpu's on-chip benchmark: cells, traffic, metric readers and the
yardstick they share (data, exact reference, trace reduction, peaks).

Run one cell from the checkout root::

    python3 benchmark/run.py --workload deep12m_ivf_flat.interactive \
        --seed 7 --seconds 20 --trace 0

The cells are listed in ``BENCHMARK.json``; each configuration, traffic
mix and per-layer metric is a file of its own under ``configs/``,
``traffic/`` and ``metrics/``, found by name.
"""
