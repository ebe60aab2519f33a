"""Atomic artifact writes for everything that is not a checkpoint.

``checkpoint.atomic_path`` owns the checkpoint/manifest commit
discipline, but it lives in a module that imports ``telemetry`` — so
telemetry exports and recordio indexes could
not reuse it without an import cycle.  This module is the stdlib-only
bottom of that stack: the same tmp + ``os.replace`` discipline with no
package imports at module scope, usable from anywhere.

The commit window (after the tmp write, before the ``os.replace``)
consults the ``artifact_write_crash`` chaos mode so the torn-write
recovery story is testable here exactly like it is for checkpoints.
"""
from __future__ import annotations

import contextlib
import os
import threading

__all__ = ["atomic_write_path"]


@contextlib.contextmanager
def atomic_write_path(path):
    """Yield a tmp path; on clean exit, ``os.replace`` it onto
    ``path``.  Readers see either the old complete file or the new
    complete file — never a torn write.  The tmp name is unique per
    (pid, thread) so concurrent writers of different targets cannot
    collide, and it is removed on every failure path."""
    path = os.fspath(path)
    tmp = "%s.tmp.%d.%d" % (path, os.getpid(),
                            threading.get_ident() % 100000)
    try:
        yield tmp
        try:
            from .parallel import chaos
        except ImportError:       # tools importing this file standalone
            chaos = None
        if chaos is not None and chaos.should_fire("artifact_write_crash"):
            raise chaos.ChaosError(
                "artifact_write_crash: crashed before commit of %r"
                % path)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
