"""Deprecated alias — the serving CLI moved to :mod:`repro.serving.cli`
(the launcher now rides on the :class:`~repro.serving.runtime.
ServingRuntime`: dynamic batching, worker pool, hot-swap; see
``docs/serving.md``).

This shim warns once on import and delegates everything — ``python -m
repro.launch.serve`` keeps working, as do the documented
:class:`InputError` / :func:`load_input_env` / :func:`main` entry points.
"""

from __future__ import annotations

import warnings

warnings.warn(
    "repro.launch.serve is deprecated: use repro.serving.cli "
    "(python -m repro.serving.cli) instead",
    DeprecationWarning, stacklevel=2)

from repro.serving.cli import (InputError, load_input_env,  # noqa: E402
                               main, serve_artifact, serve_lm)

__all__ = ["InputError", "load_input_env", "main", "serve_artifact",
           "serve_lm"]


if __name__ == "__main__":
    from repro.core.cache import enable_jax_cache
    enable_jax_cache()
    raise SystemExit(main())
