"""The benchmark of the served erasure-coding path (see README.md)."""
