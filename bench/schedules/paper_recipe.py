"""The paper's learning-rate recipe (Section V): a linear warm-up from
``base`` to ``peak`` over 10 epochs, then 1/sqrt(2) per epoch.  The
traffic file's ``lr`` holds ``base``, ``peak`` and ``steps_per_epoch``."""
import numpy as np


def for_program(lr: dict):
    """The program's own schedule object, as ``repro.launch.train.main``
    builds it."""
    from repro.optim.schedules import paper_recipe

    return paper_recipe(lr["steps_per_epoch"], base_lr=lr["base"],
                        peak_lr=lr["peak"])


def lr_at(lr: dict, k: int) -> float:
    """The reference's learning rate at step k."""
    spe = int(lr["steps_per_epoch"])
    warm = 10 * spe
    v = lr["base"] + (lr["peak"] - lr["base"]) * min(k / warm, 1.0)
    return float(np.float32(v * (2 ** -0.5) ** (max(k - warm, 0) // spe)))
