from tpu_dist.engine.checkpoint import load_checkpoint, save_checkpoint  # noqa: F401
from tpu_dist.engine.loop import Trainer  # noqa: F401
from tpu_dist.engine.state import TrainState, init_model  # noqa: F401
from tpu_dist.engine.steps import cross_entropy_sum  # noqa: F401
