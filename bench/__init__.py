"""The benchmark of the SVD system on a TPU: see bench/run.py."""
