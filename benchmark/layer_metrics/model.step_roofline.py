"""Required work over device busy time, long-document cell (compute bound):
``reduce.step_roofline``."""
from reduce import step_roofline as read  # noqa: F401
