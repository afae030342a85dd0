"""bisect_checks_per_mission.failing: combined checks below the root of
the bisection tree (cess_proof_bisect_checks_total) per mission
completed in the window; 2 log2(batch) for one failing proof."""


def read(run):
    checks = run.stages.get("cess_proof_bisect_checks_total")
    missions = run.work.get("missions")
    if checks is None or not missions:
        return None
    return checks / missions
