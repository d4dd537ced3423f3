"""Cluster and job generators of the benchmark: data from ``--seed``.

A generator module exposes ``build(cluster, shapes, seed)``, which returns the
snapshot bytes the served system restores and the plain record (numpy
arrays and lists, nothing of the program's) that the reference reads.
``jobs.make_job`` turns one job shape of a configuration file into the
struct the SDK registers and the plain record of what was asked.
"""
