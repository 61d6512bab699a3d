"""The benchmark's harness: one command runs one cell once (see run.py)."""
