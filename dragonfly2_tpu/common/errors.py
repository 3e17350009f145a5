"""Coded errors carried across RPC boundaries.

Role parity: the reference's ``internal/dferrors`` (coded errors wrapping
``commonv1.Code``) and the code constants its services switch on
(e.g. NeedBackSource / SchedulerBusy decisions in the daemon's conductor).
"""

from __future__ import annotations

import enum


class Code(enum.IntEnum):
    """Wire error codes. Stable values — part of the IDL."""

    OK = 0

    # generic
    UNKNOWN = 1000
    INVALID_ARGUMENT = 1001
    NOT_FOUND = 1002
    ALREADY_EXISTS = 1003
    PERMISSION_DENIED = 1004
    UNAVAILABLE = 1005
    DEADLINE_EXCEEDED = 1006
    RESOURCE_EXHAUSTED = 1007
    INTERNAL = 1008

    # scheduler → peer control verbs
    SCHED_NEED_BACK_SOURCE = 2000   # peer must fetch from origin itself
    SCHED_PEER_GONE = 2001          # peer was evicted; re-register
    SCHED_TASK_STATUS_ERROR = 2002  # task failed upstream
    SCHED_FORBIDDEN = 2003          # blocklisted / over limits
    SCHED_REREGISTER = 2004         # scheduler lost state; register again

    # data-plane
    CLIENT_PEER_BUSY = 2999         # parent at upload concurrency limit; not a failure
    CLIENT_PIECE_DOWNLOAD_FAIL = 3000
    CLIENT_PIECE_NOT_FOUND = 3001
    CLIENT_BACK_SOURCE_ERROR = 3002
    CLIENT_CONTEXT_CANCELED = 3003
    CLIENT_DIGEST_MISMATCH = 3004
    CLIENT_STORAGE_ERROR = 3005
    CLIENT_DEVICE_SINK_ERROR = 3006  # a requested device sink was refused or lost

    # origin
    SOURCE_ERROR = 4000
    SOURCE_NOT_FOUND = 4004
    SOURCE_RANGE_UNSUPPORTED = 4005
    SOURCE_AUTH_ERROR = 4006

    # manager / control plane
    MANAGER_STORE_ERROR = 5000
    MANAGER_KEEPALIVE_EXPIRED = 5001


class DFError(Exception):
    """An error with a wire ``Code``; survives RPC round-trips intact."""

    def __init__(self, code: Code, message: str = ""):
        super().__init__(message or code.name)
        self.code = Code(code)
        self.message = message or code.name

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"DFError({self.code.name}, {self.message!r})"

    @staticmethod
    def wrap(exc: BaseException, default: Code = Code.UNKNOWN) -> "DFError":
        if isinstance(exc, DFError):
            return exc
        return DFError(default, f"{type(exc).__name__}: {exc}")


def is_back_source(exc: BaseException) -> bool:
    return isinstance(exc, DFError) and exc.code == Code.SCHED_NEED_BACK_SOURCE
