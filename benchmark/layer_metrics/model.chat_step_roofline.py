"""Required work over device busy time, chat cells (weight-read bound):
``reduce.step_roofline``."""
from reduce import step_roofline as read  # noqa: F401
