"""The two-word lookup kernels' share of their HBM roofline, read as
``lookup_roofline_pct`` reads it: the least bytes come from
``roofline.lookup_bytes`` at the configuration's widths (at 8-byte keys
a 2 KB key row per distinct bucket, 8 bytes per key, result and hit
value), over the chip's HBM peak, over the kernels' device time."""
from pathlib import Path

from chipbench.harness import load_module

read = load_module(Path(__file__).with_name("lookup_roofline_pct.py")).read
