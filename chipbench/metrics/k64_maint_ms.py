"""Mapper replay and populate time per request over the window, of
views twice as wide at 64-bit keys and values, read as ``maint_ms``
reads it (``replay_seconds`` + ``populate_seconds``)."""
from pathlib import Path

from chipbench.harness import load_module

read = load_module(Path(__file__).with_name("maint_ms.py")).read
