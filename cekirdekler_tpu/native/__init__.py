from .build import available, load, runtime

__all__ = ["available", "load", "runtime"]
