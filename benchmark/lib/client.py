"""The load generator's wallet: funded senders and signed PayForBlobs txs.

The one place where traffic generation leans on the program: a BlobTx is
protobuf-encoded and secp256k1-signed by the program's own client library
(`client/tx_client.Signer`), as a user's wallet would. Sizes, namespaces,
contents and order come from the generators; see PERF.md, Open questions.
"""

from __future__ import annotations

GENESIS_BALANCE = 10**15


def _wallet_key(seed: bytes):
    """The program's `PrivateKey.from_seed(seed)` with its OpenSSL key object
    and public key derived once per sender and kept: the same signatures at
    a third of the time (the program derives both anew for every tx, 3.4 of
    the 6 ms a 28 KB PFB took to make; a pool of blocks is signed in every
    run's set-up)."""
    from celestia_app_tpu.chain.crypto import PrivateKey

    class WalletKey(PrivateKey):
        def _key(self):
            if "_kept_key" not in self.__dict__:
                object.__setattr__(self, "_kept_key", super()._key())
            return self.__dict__["_kept_key"]

        def public_key(self):
            if "_kept_pub" not in self.__dict__:
                object.__setattr__(self, "_kept_pub", super().public_key())
            return self.__dict__["_kept_pub"]

    return WalletKey(PrivateKey.from_seed(seed).scalar)


class Client:
    def __init__(self, chain_id: str, seed: int, senders: int):
        from celestia_app_tpu.client.tx_client import Signer

        self._signer = Signer(chain_id)
        privs = [_wallet_key(b"bench-%d-%d" % (seed, i))
                 for i in range(senders)]
        self.addresses = [self._signer.add_account(p, number=i)
                          for i, p in enumerate(privs)]
        # raw tx -> (sender address, fee, blob bytes in all its blobs): what the state check
        # and the plain validator know of a tx without parsing its signed body
        self.sent: dict[bytes, tuple[bytes, int, int]] = {}

    def genesis_accounts(self) -> list[tuple[bytes, int]]:
        return [(a, GENESIS_BALANCE) for a in self.addresses]

    def pay_for_blobs(self, sender: int, blobs: list[tuple[bytes, bytes]]
                      ) -> bytes:
        """One signed PFB of `blobs` (namespace, data) from `sender`, at its
        next sequence."""
        from celestia_app_tpu.chain.modules import estimate_pfb_gas
        from celestia_app_tpu.da.blob import Blob
        from celestia_app_tpu.da.namespace import Namespace

        addr = self.addresses[sender]
        gas = 2 * estimate_pfb_gas([len(data) for _ns, data in blobs])
        raw = self._signer.create_pay_for_blobs(
            addr, [Blob(Namespace(ns), data) for ns, data in blobs],
            fee=gas, gas_limit=gas)
        self._signer.accounts[addr].sequence += 1
        self.sent[raw] = (addr, gas, sum(len(data) for _ns, data in blobs))
        return raw
