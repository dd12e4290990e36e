"""H2 matrix core (the paper's contribution).

Public API:
    construct_h2          kernel + points -> (H2Shape, H2Data)
    construct_h2_host     the same with host (numpy) factors
    h2_matvec             y = A x (multi-vector)
    orthogonalize         basis orthogonalization (upsweep QR)
    compress              algebraic recompression (paper §5)
    partition_h2          block-row decomposition for P devices (host)
    distribute_h2         partition_h2 + each block row onto its device
    make_dist_matvec      shard_map distributed matvec
    make_dist_compress    shard_map distributed recompression to a tol
"""
from .structure import (H2Shape, H2Data, CouplingPlan, abstract_data,  # noqa
                        build_coupling_plan, remarshal, shape_of)
from .construction import (construct_h2, construct_h2_host,      # noqa
                           dense_reference)
from .matvec import h2_matvec, h2_matvec_flops                    # noqa
from .orthogonalize import orthogonalize                          # noqa
from .compression import compress                                 # noqa
from .halo import HaloPlan                                        # noqa
from .dist import (partition_h2, distribute_h2, make_dist_matvec,  # noqa
                   make_dist_compress, matvec_comm_bytes)
