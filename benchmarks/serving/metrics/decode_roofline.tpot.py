"""decode_roofline.tpot: see ``readers.decode_roofline``."""
from readers import decode_roofline as read  # noqa: F401
