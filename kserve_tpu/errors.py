"""Typed exceptions shared by the data plane and protocol layers.

Parity: reference python/kserve/kserve/errors.py (exception hierarchy and the
HTTP status codes each maps to); re-implemented for an aiohttp-based stack.
"""

from __future__ import annotations


class InferenceError(RuntimeError):
    """Raised by a model when inference itself fails (HTTP 500)."""

    def __init__(self, reason: str, status: str | None = None, debug_info: str | None = None):
        self.reason = reason
        self.status = status
        self.debug_info = debug_info
        super().__init__(reason)

    def __str__(self) -> str:
        msg = self.reason
        if self.status:
            msg = f"{msg}, status: {self.status}"
        if self.debug_info:
            msg = f"{msg}, debug: {self.debug_info}"
        return msg


class InvalidInput(ValueError):
    """Raised when the request payload fails validation (HTTP 400)."""

    def __init__(self, reason: str):
        self.reason = reason
        super().__init__(reason)


class ModelNotFound(Exception):
    """Raised when the named model is not in the repository (HTTP 404)."""

    def __init__(self, model_name: str | None = None):
        self.model_name = model_name
        self.reason = f"Model with name {model_name} does not exist."
        super().__init__(self.reason)


class ModelNotReady(RuntimeError):
    """Raised when the model exists but has not finished loading (HTTP 503)."""

    def __init__(self, model_name: str, detail: str | None = None):
        self.model_name = model_name
        self.error_msg = f"Model with name {model_name} is not ready."
        if detail:
            self.error_msg = self.error_msg + " " + detail
        super().__init__(self.error_msg)


class ServerNotReady(RuntimeError):
    """Raised when the server as a whole is not ready (HTTP 503)."""

    def __init__(self, detail: str | None = None):
        self.error_msg = detail or "Server is not ready."
        super().__init__(self.error_msg)


class ServerNotLive(RuntimeError):
    def __init__(self, detail: str | None = None):
        self.error_msg = detail or "Server is not live."
        super().__init__(self.error_msg)


class UnsupportedProtocol(Exception):
    def __init__(self, protocol_version: str):
        self.reason = f"Unsupported protocol {protocol_version}."
        super().__init__(self.reason)


class NoModelReady(RuntimeError):
    def __init__(self, models: list):
        self.models = models
        super().__init__()

    def __str__(self) -> str:
        names = [getattr(m, "name", str(m)) for m in self.models]
        return f"Models with name {','.join(names)} are not ready."
