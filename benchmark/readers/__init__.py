"""Per-layer readers: ``read(ctx)`` takes one metric from the run's spans,
counters or reduced trace, and returns ``None`` where it finds nothing to
read (the harness then leaves the metric out of the line). A metric
``<base>.<suffix>`` is read by ``readers/<base>.py``."""
