"""The benchmark: harness, traffic, arithmetic, trace reduction, plain
references and the comparison that decides ``correct``.  See PERF.md."""
