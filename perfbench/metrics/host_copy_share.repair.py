"""host_copy_share.repair: percent of the window in RSStream's host
memory copies: the group gather, padded and contiguous copies before an
upload, and the concatenate and scatter after the pulls
(cess_rs_{gather,scatter}_seconds, the spans `cess.rs.gather` and
`cess.rs.scatter`)."""

from harness.readers import stage_share


def read(run):
    return stage_share(run, ["cess_rs_gather_seconds",
                             "cess_rs_scatter_seconds"])
