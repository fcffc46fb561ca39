-- define [NULLCOLSS] = choice('ss_customer_sk','ss_cdemo_sk','ss_hdemo_sk','ss_addr_sk','ss_store_sk','ss_promo_sk')
-- define [NULLCOLWS] = choice('ws_bill_customer_sk','ws_bill_hdemo_sk','ws_bill_addr_sk','ws_ship_customer_sk','ws_ship_cdemo_sk','ws_ship_hdemo_sk','ws_ship_addr_sk','ws_web_page_sk','ws_web_site_sk','ws_ship_mode_sk','ws_warehouse_sk','ws_promo_sk')
-- define [NULLCOLCS] = choice('cs_bill_customer_sk','cs_bill_hdemo_sk','cs_bill_addr_sk','cs_ship_customer_sk','cs_ship_cdemo_sk','cs_ship_hdemo_sk','cs_ship_addr_sk','cs_ship_mode_sk','cs_warehouse_sk','cs_promo_sk')
SELECT channel, col_name, d_year, d_qoy, i_category,
       COUNT(*) AS sales_cnt, SUM(ext_sales_price) AS sales_amt
FROM (SELECT 'store' AS channel, '[NULLCOLSS]' AS col_name, d_year, d_qoy,
             i_category, ss_ext_sales_price AS ext_sales_price
      FROM store_sales, item, date_dim
      WHERE [NULLCOLSS] IS NULL
        AND ss_sold_date_sk = d_date_sk
        AND ss_item_sk = i_item_sk
      UNION ALL
      SELECT 'web' AS channel, '[NULLCOLWS]' AS col_name, d_year,
             d_qoy, i_category, ws_ext_sales_price AS ext_sales_price
      FROM web_sales, item, date_dim
      WHERE [NULLCOLWS] IS NULL
        AND ws_sold_date_sk = d_date_sk
        AND ws_item_sk = i_item_sk
      UNION ALL
      SELECT 'catalog' AS channel, '[NULLCOLCS]' AS col_name, d_year,
             d_qoy, i_category, cs_ext_sales_price AS ext_sales_price
      FROM catalog_sales, item, date_dim
      WHERE [NULLCOLCS] IS NULL
        AND cs_sold_date_sk = d_date_sk
        AND cs_item_sk = i_item_sk) foo
GROUP BY channel, col_name, d_year, d_qoy, i_category
ORDER BY channel, col_name, d_year, d_qoy, i_category
LIMIT 100
