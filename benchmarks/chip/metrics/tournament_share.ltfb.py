"""Share of the training window spent in tournaments (harness clock
around each ``TournamentOrchestrator.tournament()`` call)."""


def read(obs):
    if not obs.get("window_s"):
        return None
    return 100.0 * obs["tournament_s"] / obs["window_s"]
