"""How a workload's jobs are offered: one module a loop kind, found by
the workload file's ``loop``."""
