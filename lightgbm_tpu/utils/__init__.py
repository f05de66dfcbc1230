from . import log

__all__ = ["log"]
