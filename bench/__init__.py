"""Chip benchmark of the BERT pre-training path; the entry is bench/run.py."""
