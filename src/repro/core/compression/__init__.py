from repro.core.compression.plan import (CompressionPlan, DEVICE_TIERS,
                                         plan_arrays, default_tier_plans)  # noqa: F401
from repro.core.compression.pruning import magnitude_mask  # noqa: F401
from repro.core.compression.quantization import fake_quant_ste  # noqa: F401
from repro.core.compression.clustering import (cluster_ste,
                                               kmeans_codebook)  # noqa: F401
from repro.core.compression.structured import (SubmodelSpec, expand_masks,
                                               expand_update, slice_submodel,
                                               slice_tree,
                                               submodel_spec)  # noqa: F401
from repro.core.compression.apply import (active_param_count, compress_params,
                                          compress_with_masks, compressible,
                                          payload_bits,
                                          tier_thresholds)  # noqa: F401
