from .log import Log  # noqa: F401
