"""decode_hbm_share.rate: the reading of ``decode_hbm_share`` in the
open-loop cells, where a decode step's time sets the gap between tokens.
Same trace names (``jit_serve_step``)."""
from pathlib import Path

from cells import load_module

LAYER = "engine"
MOVES = "itl_p95_ms"
MATCHES = ("jit_serve_step",)

read = load_module(Path(__file__).with_name("decode_hbm_share.py")).read
