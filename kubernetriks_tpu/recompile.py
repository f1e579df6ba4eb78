"""Recompile sentinel: the runtime cross-check of the fleet's
compile-once guarantee (`KTPU_EXPLAIN_RECOMPILES`).

The static half is the scenariotrace lint pass (a scenario leaf can
never flow into program-shaping positions); the dynamic half is
jit-cache-size equality (`fleet.jit_cache_sizes`, tests/test_fleet.py,
tests/test_fleet_async.py, tests/test_fleet_faults.py).
Both tell you THAT something recompiled — neither names WHICH jit entry
did. This module hooks `jax_log_compiles` (every XLA compilation logs
"Finished XLA compilation of <entry> in ... sec" on the
`jax._src.dispatch` logger) and turns post-warm-up compilations into a
`RecompileError` (or warning) carrying the entry names, so a
shape-drifting call or a scenario parameter that regressed to a
jit-static is diagnosed in one line instead of a cache-count diff.

Usage (the fleet and chip_smoke.py's served leg wire this up):

    sent = RecompileSentinel().install()
    ...build + warm up...
    sent.seal("warm-up done")           # compiles beyond here are events
    ...steady state...
    sent.check("query stream")          # raises/warns, naming entries
    sent.uninstall()

or windowed, immune to neighboring engines compiling in between:

    with sent.expect_none("fleet wave 3"):
        ...one wave...

`KTPU_EXPLAIN_RECOMPILES` (tristate): 1 -> `ScenarioFleet` arms a raising
sentinel around every post-warm-up wave; unset or 0 -> it arms none (code
that installs a sentinel of its own, as chip_smoke.py does, is not asked).

The log hook silences the two jax compile loggers' propagation while
installed (their WARNING-level spam would otherwise hit stderr on every
legitimate warm-up compile) and restores both the propagation and the
`jax_log_compiles` setting on uninstall. Nesting is supported; the
handler stays attached until the last sentinel uninstalls.
"""

from __future__ import annotations

import logging
import threading
import warnings
from typing import List, Optional

from kubernetriks_tpu.flags import flag_tristate
from kubernetriks_tpu.telemetry.tracer import recorder

_COMPILE_LOGGER = "jax._src.dispatch"
# pxla's "Compiling <fn> with global shapes..." WARNING rides a second
# logger; silenced alongside (it duplicates the dispatch signal).
_NOISE_LOGGERS = ("jax._src.interpreters.pxla",)
_PREFIX = "Finished XLA compilation of "


class RecompileError(RuntimeError):
    """A jit entry compiled after the sentinel was sealed."""


class RecompileWarning(RuntimeWarning):
    pass


class _CompileLogHandler(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.lock2 = threading.Lock()
        self.sentinels: List["RecompileSentinel"] = []

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
            if not msg.startswith(_PREFIX):
                return
            name, _, took = msg[len(_PREFIX) :].rpartition(" in ")
            with self.lock2:
                for sent in self.sentinels:
                    sent._events.append(name)
            # One `compile` span per program on the process-wide recorder
            # (a persistent-cache load logs the same line with its load
            # time): what set-up's compile_or_load_s sums.
            recorder().compile_event(name, float(took.split()[0]))
        except Exception:  # a telemetry hook must never break dispatch
            pass


_HANDLER = _CompileLogHandler()
_INSTALL_LOCK = threading.Lock()
_SAVED_STATE: dict = {}


def _attach() -> None:
    import jax

    _SAVED_STATE["log_compiles"] = bool(jax.config.jax_log_compiles)
    _SAVED_STATE["propagate"] = {
        name: logging.getLogger(name).propagate
        for name in (_COMPILE_LOGGER,) + _NOISE_LOGGERS
    }
    jax.config.update("jax_log_compiles", True)
    # The handler rides EVERY compile logger: on the dispatch logger it
    # collects events; on the noise loggers it only exists so the record
    # finds a handler — propagate=False alone would still reach
    # logging.lastResort (stderr) on handler-less loggers.
    for name in (_COMPILE_LOGGER,) + _NOISE_LOGGERS:
        logger = logging.getLogger(name)
        logger.addHandler(_HANDLER)
        logger.propagate = False


def _detach() -> None:
    import jax

    for name in (_COMPILE_LOGGER,) + _NOISE_LOGGERS:
        logging.getLogger(name).removeHandler(_HANDLER)
    for name, prop in _SAVED_STATE.get("propagate", {}).items():
        logging.getLogger(name).propagate = prop
    jax.config.update(
        "jax_log_compiles", _SAVED_STATE.get("log_compiles", False)
    )


class RecompileSentinel:
    """Collects XLA-compilation events and enforces a zero-recompile
    contract past a seal point (or inside expect_none windows)."""

    def __init__(self, mode: str = "raise"):
        if mode not in ("raise", "warn"):
            raise ValueError(f"mode must be 'raise' or 'warn', got {mode!r}")
        self.mode = mode
        self._events: List[str] = []
        self._sealed_at: Optional[int] = None
        self._installed = False

    # -- lifecycle ---------------------------------------------------------

    def install(self) -> "RecompileSentinel":
        with _INSTALL_LOCK:
            if not self._installed:
                if not _HANDLER.sentinels:
                    _attach()
                with _HANDLER.lock2:
                    _HANDLER.sentinels.append(self)
                self._installed = True
        return self

    def uninstall(self) -> None:
        with _INSTALL_LOCK:
            if self._installed:
                with _HANDLER.lock2:
                    if self in _HANDLER.sentinels:
                        _HANDLER.sentinels.remove(self)
                self._installed = False
                if not _HANDLER.sentinels:
                    _detach()

    def __enter__(self) -> "RecompileSentinel":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # -- the contract ------------------------------------------------------

    @property
    def events(self) -> List[str]:
        """Entry names of every compilation observed since install()."""
        with _HANDLER.lock2:
            return list(self._events)

    def seal(self, context: str = "warm-up") -> None:
        """Mark the end of warm-up: compilations beyond this point are
        contract violations for check()."""
        with _HANDLER.lock2:
            self._sealed_at = len(self._events)

    def post_seal_events(self) -> List[str]:
        with _HANDLER.lock2:
            if self._sealed_at is None:
                return []
            return list(self._events[self._sealed_at :])

    def _report(self, names: List[str], context: str) -> None:
        listing = ", ".join(sorted(set(names)))
        msg = (
            f"KTPU_EXPLAIN_RECOMPILES: {len(names)} post-warm-up XLA "
            f"compilation(s) during {context or 'the sealed region'} — "
            f"jit entries: {listing}. A traced input's shape/dtype "
            "drifted or a parameter regressed to a jit-static; the "
            "compile-once contract is broken."
        )
        if self.mode == "raise":
            raise RecompileError(msg)
        warnings.warn(msg, RecompileWarning, stacklevel=3)

    def check(self, context: str = "") -> None:
        """Raise (or warn) if anything compiled since seal()."""
        names = self.post_seal_events()
        if names:
            # Re-seal so a warn-mode caller is not re-warned forever.
            self.seal()
            self._report(names, context)

    def expect_none(self, context: str):
        """Context manager: no compilation may happen inside the block
        (independent of seal(), so neighboring engines compiling between
        blocks don't contaminate the verdict)."""
        sentinel = self

        class _Window:
            def __enter__(self_w):
                with _HANDLER.lock2:
                    self_w.start = len(sentinel._events)
                return sentinel

            def __exit__(self_w, exc_type, exc, tb):
                if exc_type is not None:
                    return False
                with _HANDLER.lock2:
                    names = list(sentinel._events[self_w.start :])
                if names:
                    sentinel._report(names, context)
                return False

        return _Window()


def sentinel_mode() -> Optional[bool]:
    """The KTPU_EXPLAIN_RECOMPILES tristate: True -> the fleet arms a
    raising sentinel; None (unset) or False -> it does not."""
    return flag_tristate("KTPU_EXPLAIN_RECOMPILES")


def maybe_sentinel() -> Optional[RecompileSentinel]:
    """An installed raising sentinel when the flag is explicitly on
    (ScenarioFleet's wiring), else None."""
    if sentinel_mode() is True:
        return RecompileSentinel("raise").install()
    return None
