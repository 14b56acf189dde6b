"""Shared pytest config.

NOTE (assignment): XLA_FLAGS / host-device-count is deliberately NOT set
here — smoke tests must see the default single CPU device; the 512-device
dry-run paths run in subprocesses (tests/test_launch.py).

A persistent compilation cache keeps repeated full-suite runs fast (the
unrolled FL round programs dominate compile time otherwise); see
``repro.compile_cache`` for where it lives.
"""
from repro.compile_cache import enable_compile_cache

enable_compile_cache()
