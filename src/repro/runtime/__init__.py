from .elastic import choose_mesh_shape, reshard  # noqa: F401
from .failures import ChaosError, FailureInjector  # noqa: F401
from .watchdog import StepWatchdog  # noqa: F401
from . import spans  # noqa: F401
