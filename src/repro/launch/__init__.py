"""Launchers: the device mesh (`mesh`) and the train/serve CLIs."""
from . import mesh

__all__ = ["mesh"]
