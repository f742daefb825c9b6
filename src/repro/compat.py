"""Deprecation category for the repo's own compatibility shims.

The repo targets one jax (``requirements-dev.txt``) and calls its API
directly; this module only holds the warning class its deprecated entry
points raise.
"""
from __future__ import annotations


class ReproDeprecationWarning(DeprecationWarning):
    """Deprecation of a *repro* entry point (shims kept for API compat).

    A dedicated subclass so CI can escalate exactly the in-repo shims to
    errors (``-W error::repro.compat.ReproDeprecationWarning``) without
    also erroring on third-party DeprecationWarnings — the plain-category
    ``module`` filter cannot do this, because our shims warn with
    ``stacklevel=2`` and therefore attribute the warning to the *caller's*
    module, not ``repro.*``.
    """
