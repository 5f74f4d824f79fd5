"""Device time of the lookup kernels per request at 64-bit keys and
values (the two-word kernels under the same jit names), read as
``lookup_kernel_ms`` reads it."""
from pathlib import Path

from chipbench.harness import load_module

read = load_module(Path(__file__).with_name("lookup_kernel_ms.py")).read
