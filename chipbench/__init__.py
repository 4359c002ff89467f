"""On-chip benchmark of the stream->train and stream->serve paths.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once; see ``chipbench/README.md``.
"""
