"""An always-empty tuning database, kept only for callers outside the program.

Kernel choice comes from the cost gate alone (:mod:`repro.core.routing`);
nothing in the program reads or writes a tuning database.  The benchmark
harness still imports :func:`default_tuning_db` to refuse a populated one,
so this stub stays until that guard is dropped.
"""

from __future__ import annotations

from types import MappingProxyType, SimpleNamespace


def default_tuning_db() -> SimpleNamespace:
    """A database with no entries: ``entries`` is an empty mapping."""
    return SimpleNamespace(entries=MappingProxyType({}))


__all__ = ["default_tuning_db"]
