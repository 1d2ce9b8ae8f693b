"""Resolve-once boolean environment flags with override hooks.

The engine_lint ``env-read`` contract: an ``os.environ`` read belongs
at import/construction time or behind a resolve-once helper — never in
a per-page/per-query path (a dict lookup per page, and program choice
that flips mid-process with the environment).  Every boolean switch
(``PRESTO_TPU_EXCHANGE_STREAMING``, ``PRESTO_TPU_VALIDATE_PLANS``, ...)
shares this one implementation instead of hand-rolling the
getter/setter pair.

Usage::

    _VALIDATION = EnvFlag("PRESTO_TPU_VALIDATE_PLANS", default=False)
    if _VALIDATION(): ...
    _VALIDATION.set(True)   # test override; .set(None) re-resolves
"""

from __future__ import annotations

from typing import Optional


def _resolve_env_flag(name: str, default: bool) -> bool:
    import os

    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in ("", "0", "false")


def _resolve_env_int(name: str, default: int) -> int:
    import os

    raw = os.environ.get(name)
    try:
        return default if raw is None or not raw.strip() else int(raw)
    except ValueError:
        return default


class EnvFlag:
    """A boolean env var resolved ONCE per process, with an override
    hook for tests/tools (``set(True/False)``; ``set(None)``
    re-resolves from the environment on next read)."""

    __slots__ = ("name", "default", "_value")

    def __init__(self, name: str, default: bool = True):
        self.name = name
        self.default = default
        self._value: Optional[bool] = None

    def __call__(self) -> bool:
        if self._value is None:
            self._value = _resolve_env_flag(self.name, self.default)
        return self._value

    def set(self, value: Optional[bool]) -> None:
        self._value = value


class EnvInt:
    """An integer env var resolved ONCE per process, same contract as
    :class:`EnvFlag` (``set(n)`` overrides; ``set(None)`` re-resolves).
    Values clamp to ``floor`` so a malformed/negative setting can never
    produce an unbounded or zero-width pool."""

    __slots__ = ("name", "default", "floor", "_value")

    def __init__(self, name: str, default: int, floor: int = 0):
        self.name = name
        self.default = int(default)
        self.floor = int(floor)
        self._value: Optional[int] = None

    def __call__(self) -> int:
        if self._value is None:
            self._value = max(self.floor,
                              _resolve_env_int(self.name, self.default))
        return self._value

    def set(self, value: Optional[int]) -> None:
        self._value = None if value is None else max(self.floor, int(value))
