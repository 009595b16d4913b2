"""Compulsory work of each format's kernels, by format name."""
