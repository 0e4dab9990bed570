"""Model architectures (registered in the ``architectures`` registry)."""

from .core import Model, Context, chain, residual, clone, count_params, param_paths  # noqa: F401
from . import layers  # noqa: F401
from . import tok2vec  # noqa: F401  (registers spacy.HashEmbedCNN.v2 etc.)
from . import heads  # noqa: F401  (registers spacy.Tagger.v2 etc.)
from . import parser  # noqa: F401  (registers spacy.TransitionBasedParser.v2)
from . import transformer  # noqa: F401  (registers spacy_ray_tpu.TransformerEncoder.v1)
from . import latent_moe  # noqa: F401  (registers spacy_ray_tpu.LatentMoETrunk.v1)
from . import hybrid_ssm  # noqa: F401  (registers spacy_ray_tpu.HybridSSMTrunk.v1)
