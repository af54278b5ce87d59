"""Mean milliseconds of one ``ScoringEngine.step`` with work in the
traced part of a below-capacity window (``lib.readers.mean_step_ms``)."""
from lib.readers import mean_step_ms as read  # noqa: F401
