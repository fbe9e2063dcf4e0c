from paddlebox_tpu.models.ctr_dnn import CtrDnn
from paddlebox_tpu.models.deepfm import DeepFM
from paddlebox_tpu.models.wide_deep import WideDeep
from paddlebox_tpu.models.dcn import DCNv2
from paddlebox_tpu.models.ads_rank import AdsRank
from paddlebox_tpu.models.mmoe import MMoE, MMoESingle
from paddlebox_tpu.models.nemotron_h import NemotronH
from paddlebox_tpu.models.lfm2 import Lfm2Moe
from paddlebox_tpu.models.mellum import MellumMoe
from paddlebox_tpu.models.ouro import OuroLoop
from paddlebox_tpu.models.kimi_linear import KimiLinear
from paddlebox_tpu.models.laguna import LagunaMoe

MODEL_REGISTRY = {
    "ctr_dnn": CtrDnn,
    "deepfm": DeepFM,
    "wide_deep": WideDeep,
    "dcn_v2": DCNv2,
    "ads_rank": AdsRank,
    "mmoe": MMoESingle,
}

__all__ = ["CtrDnn", "DeepFM", "WideDeep", "DCNv2", "AdsRank",
           "MMoE", "MMoESingle", "NemotronH", "Lfm2Moe", "MellumMoe",
           "OuroLoop", "KimiLinear", "LagunaMoe", "MODEL_REGISTRY"]
