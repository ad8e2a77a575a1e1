from photon_tpu.game.config import (  # noqa: F401
    FixedEffectCoordinateConfig,
    MatrixFactorizationCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_tpu.game.data import CSRMatrix, DenseMatrix, GameData  # noqa: F401
from photon_tpu.game.estimator import BuiltFit, GameEstimator  # noqa: F401
from photon_tpu.game.model import (  # noqa: F401
    FixedEffectModel,
    GameModel,
    MatrixFactorizationModel,
    RandomEffectModel,
)
from photon_tpu.game.scoring import GameScorer  # noqa: F401
from photon_tpu.game.transformer import GameTransformer  # noqa: F401
