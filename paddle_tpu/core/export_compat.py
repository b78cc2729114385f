"""Call-time access to ``jax.export``.

Export consumers resolve the module through here at CALL time, so
importing them never imports ``jax.export``:

    from ..core.export_compat import get_jax_export
    exp = get_jax_export().export(jax.jit(fn))(*specs)

The installed jax always has the module; the availability names below
stay because callers and tests import them.
"""
from __future__ import annotations

__all__ = ["ExportUnavailableError", "get_jax_export",
           "jax_export_available"]


class ExportUnavailableError(ImportError):
    """A jax build without jax.export (not the installed one)."""


def get_jax_export():
    """The jax.export module."""
    import jax.export as je

    return je


def jax_export_available() -> bool:
    return True
