"""rs_host_share.repair: percent of the window in RSStream host pack
and unpack (cess_rs_{pack,unpack}_seconds)."""

from harness.readers import stage_share


def read(run):
    return stage_share(run, ["cess_rs_pack_seconds", "cess_rs_unpack_seconds"])
