"""Share of the traced window in which no operation ran on the device,
in a serving cell below capacity (``lib.readers.idle_share``)."""
from lib.readers import idle_share as read  # noqa: F401
