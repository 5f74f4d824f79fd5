"""Device time of the two-word insert (``jit(eh_insert_many)``) per
request, read as ``insert_device_ms`` reads it."""
from pathlib import Path

from chipbench.harness import load_module

read = load_module(Path(__file__).with_name("insert_device_ms.py")).read
