"""Serving layer user contract.

Reference: framework/oryx-api/src/main/java/com/cloudera/oryx/api/serving/
ServingModelManager.java:35-76, ServingModel.java:23,
AbstractServingModelManager.java:35, OryxServingException.java:26,
HasCSV.java:25.
"""

from __future__ import annotations

import abc
from typing import Any, Iterator

from ..common.config import Config
from ..kafka.api import KeyMessage

__all__ = [
    "ServingModel", "ServingModelManager", "AbstractServingModelManager",
    "StaticModelManager", "OryxServingException", "HasCSV",
]


class ServingModel(abc.ABC):
    """In-memory model state of the serving layer."""

    @abc.abstractmethod
    def get_fraction_loaded(self) -> float: ...


class ServingModelManager(abc.ABC):
    """Consumes models/updates from the update topic and exposes the
    current servable model.  Configured via
    ``oryx.serving.model-manager-class``."""

    @abc.abstractmethod
    def consume(self, updates: Iterator[KeyMessage]) -> None: ...

    @abc.abstractmethod
    def get_model(self) -> Any: ...

    def get_config(self) -> Config | None:
        return None

    def is_read_only(self) -> bool:
        return False

    def close(self) -> None:
        pass


class AbstractServingModelManager(ServingModelManager):
    """Adapts the stream contract to a per-message callback
    (reference: AbstractServingModelManager.java:35)."""

    def __init__(self, config: Config):
        self._config = config
        self._read_only = config.get_bool("oryx.serving.api.read-only")

    def get_config(self) -> Config:
        return self._config

    def is_read_only(self) -> bool:
        return self._read_only

    def consume(self, updates: Iterator[KeyMessage]) -> None:
        for km in updates:
            self.consume_key_message(km.key, km.message)

    @abc.abstractmethod
    def consume_key_message(self, key: str | None, message: str) -> None: ...


class StaticModelManager(ServingModelManager):
    """Read-only manager serving a prebuilt model, for endpoint tests and
    dry runs (reference test scope: MockServingModelManager.java:27).
    Subclass per use and set the ``model`` class attribute."""

    model = None

    def __init__(self, config=None):
        pass

    def consume(self, updates) -> None:
        pass

    def get_model(self):
        return type(self).model

    def is_read_only(self) -> bool:
        return True


class OryxServingException(Exception):
    """An error with an HTTP status, mapped to a plain-text error response
    (reference: OryxServingException.java:26).  ``headers`` optionally
    rides extra response headers out with the error page — the write
    path's shed responses carry ``Retry-After`` this way."""

    def __init__(self, status: int, message: str = "",
                 headers: dict | None = None):
        super().__init__(message)
        self.status = status
        self.headers = headers


class HasCSV(abc.ABC):
    """Response DTOs that know how to render as a CSV line
    (reference: HasCSV.java:25)."""

    @abc.abstractmethod
    def to_csv(self) -> str: ...
