"""Benchmark of the KubePACS decision plane on the TPU (see ``run.py``)."""
