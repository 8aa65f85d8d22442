"""The part of the state machine that PayForBlobs traffic moves, plainly:
auth sequences, bank balances, fees, and the mint's block provision.

Upstream's rules (celestia-app x/mint: minter.go, constants.go; the ante's
fee deduction): a block's BeginBlock mints annual_provisions * elapsed /
year to the fee collector, with annual_provisions = inflation * supply and
inflation 8% falling by a tenth each year to a floor of 1.5%, nothing in the
first block; a delivered tx raises its sender's sequence by one and moves its
fee from the sender to the fee collector, from where distribution moves it
on to the reward pool. All in whole utia, rounding down. Nothing of the
program is imported here.
"""

from __future__ import annotations

import hashlib

PPM = 1_000_000
SECONDS_PER_YEAR = 31_556_952          # 365.2425 days
INITIAL_INFLATION_PPM = 80_000
TARGET_INFLATION_PPM = 15_000
POWER_REDUCTION = 1_000_000            # utia of stake to one unit of power


class Ledger:
    def __init__(self, accounts: list[tuple[bytes, int]],
                 validator_power: int):
        self.accounts = {a: [0, b] for a, b in accounts}   # [sequence, utia]
        # a genesis validator's power is bonded stake, minted with the chain
        self.supply = (sum(b for _a, b in accounts)
                       + validator_power * POWER_REDUCTION)
        self.fees_and_rewards = 0      # fee collector + reward pool together
        self._genesis_time: int | None = None
        self._previous_time = 0

    def begin_block(self, time_unix: int) -> int:
        if self._genesis_time is None:
            self._genesis_time = self._previous_time = time_unix
            return 0
        years = (time_unix - self._genesis_time) // SECONDS_PER_YEAR
        inflation = max(INITIAL_INFLATION_PPM * 9 ** years // 10 ** years,
                        TARGET_INFLATION_PPM)
        annual = inflation * self.supply // PPM
        elapsed = max(0, time_unix - self._previous_time)
        provision = annual * elapsed // SECONDS_PER_YEAR
        self.supply += provision
        self.fees_and_rewards += provision
        self._previous_time = time_unix
        return provision

    def deliver(self, sender: bytes, fee: int, collect: bool = True) -> None:
        account = self.accounts[sender]
        account[0] += 1
        account[1] -= fee
        if collect:
            self.fees_and_rewards += fee

    def totals(self) -> dict[str, int]:
        return {"supply": self.supply,
                "fees_and_rewards": self.fees_and_rewards}

    def state_hash(self) -> bytes:
        """A hash of this ledger (the plain validator's app hash: its own
        encoding, compared with nothing of the program's)."""
        h = hashlib.sha256(b"%d/%d/%d" % (self.supply, self.fees_and_rewards,
                                          self._previous_time))
        for address in sorted(self.accounts):
            h.update(address + b"%d/%d" % tuple(self.accounts[address]))
        return h.digest()
