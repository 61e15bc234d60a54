"""queue_wait_p50: see ``readers.queue_wait_p50``."""
from readers import queue_wait_p50 as read  # noqa: F401
