"""A spine-leaf rack of PBR switches as the program builds it: hosts on one
side of the fabric, memory expanders on the other, ``per_leaf`` of each on
a leaf switch, every leaf linked to every spine (`topology.spine_leaf`)."""


def topology(C, cfg: dict):
    fab, link, ep = cfg["fabric"], cfg["link"], cfg["endpoint"]
    endpoint = C.EndpointSpec(
        bw_MBps=int(ep["bw_MBps"]), fixed_ps=int(ep["fixed_ps"]),
        banks=int(ep["banks"]),
        row_hit_extra_ps=int(ep["row_hit_extra_ps"]),
        row_miss_extra_ps=int(ep["row_miss_extra_ps"]),
        lines_per_row=int(ep["lines_per_row"]))
    topo = C.spine_leaf(int(fab["n_pairs"]), n_spines=int(fab["n_spines"]),
                        per_leaf=int(fab["per_leaf"]),
                        bw_MBps=int(link["bw_MBps"]),
                        fixed_ps=int(link["fixed_ps"]), endpoint=endpoint,
                        switching_ps=int(cfg["switching_ps"]))
    return C.with_flit(topo, link["flit"])
