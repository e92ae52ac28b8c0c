"""Roofline share of the MH sampling step in a batch cell, in %
(see ``_roofline.py``); read where the update rule is MH."""

import lib
from pathlib import Path

_share = lib.load_module(Path(__file__).with_name("_roofline.py")).share


def read(r):
    return _share(r) if r.cfg.get("update") == "mh" else None
