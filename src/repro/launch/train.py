"""Deprecated alias — the training CLI moved to :mod:`repro.training.cli`
(the launcher now also drives the graph-level-autodiff
:class:`~repro.api.CompiledTrainStep` via ``--compiled``; see
``docs/autodiff.md``).

This shim warns once on import and delegates everything — ``python -m
repro.launch.train`` keeps working, as does the documented :func:`main`
entry point.
"""

from __future__ import annotations

import warnings

warnings.warn(
    "repro.launch.train is deprecated: use repro.training.cli "
    "(python -m repro.training.cli) instead",
    DeprecationWarning, stacklevel=2)

from repro.training.cli import main  # noqa: E402

__all__ = ["main"]


if __name__ == "__main__":
    from repro.core.cache import enable_jax_cache
    enable_jax_cache()
    raise SystemExit(main())
