"""decode_roofline.tput: see ``readers.decode_roofline``."""
from readers import decode_roofline as read  # noqa: F401
