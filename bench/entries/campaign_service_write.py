"""Entry: ``CampaignService.submit``, as ``campaign_service``, with the
traffic's own check (``check`` in the mix) in place of the
configuration's: a write stream's answers are checked against the write
reference, on the configuration of the read cells."""
from bench import harness

_service_entry = harness.load_module("entries", "campaign_service")


class Entry(_service_entry.Entry):
    def __init__(self, config: dict, traffic: dict):
        super().__init__(dict(config, check=traffic["check"]), traffic)
